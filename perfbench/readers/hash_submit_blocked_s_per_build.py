"""Seconds per counted build that building threads were blocked in
``HashService.submit``: growth of
``makisu_commit_stage_busy_seconds{stage="service_submit"}``
(``chunker/cdc.py:_emit``), the backpressure of the shared hash
service's queues (two batches' worth of chunks a bucket) alone.
``hash_service_wait_s_per_build`` holds these seconds too, with the
wait for the futures at ``finish()``: the difference is that wait.
``None`` from a program without the stage."""
from pbharness import hostspans


def read(run):
    return hostspans.counter_per_build(
        run, "makisu_commit_stage_busy_seconds", stage="service_submit")
