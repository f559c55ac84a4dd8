"""Seconds per counted build that a building thread was blocked on one
of its sink's reader threads while it wrote a layer's tar: growth of
``makisu_commit_stage_busy_seconds{stage="read_wait"}`` (the native
sink's ``lsk_prefetch_stats``, added once a layer at the sink's finish:
the waits for a file a reader had not finished when the writer needed
it). A part of ``tar_write_s_per_build``; a file the writer streams
itself is not in it. ``None`` from a program whose sink has no
readers."""
from pbharness import hostspans


def read(run):
    return hostspans.counter_per_build(
        run, "makisu_commit_stage_busy_seconds", stage="read_wait")
