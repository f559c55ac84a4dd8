"""Seconds per counted build of the host's own work in the device
feed: growth of ``makisu_commit_stage_busy_seconds`` for the stages
``gear_dispatch`` (block staging and the scan's dispatch), ``host_cut``
(bit unpack, the cut policy, lane packing) and ``sha_dispatch``. What
``device_wait_s_per_build`` leaves out of the feed."""
from pbharness import hostspans

_STAGES = ("gear_dispatch", "host_cut", "sha_dispatch")


def read(run):
    busy = [hostspans.counter_per_build(
        run, "makisu_commit_stage_busy_seconds", stage=stage)
        for stage in _STAGES]
    if all(b is None for b in busy):
        return None
    return sum(b or 0.0 for b in busy)
