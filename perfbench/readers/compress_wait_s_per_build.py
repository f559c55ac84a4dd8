"""Seconds per counted build that a building thread was blocked on its
layer's gzip stream: growth of
``makisu_commit_stage_busy_seconds{stage="compress_wait"}`` (the native
sink's ring full under ``tar_write``, then the drain under
``sink_finish``). Beside ``compress_s_per_build`` it says which side of
the commit is the brake: near 0 where the producer is slower than
gzip, near ``compress`` less the producer's own seconds where gzip is.
``None`` from a program whose sink deflates in line."""
from pbharness import hostspans


def read(run):
    return hostspans.counter_per_build(
        run, "makisu_commit_stage_busy_seconds", stage="compress_wait")
