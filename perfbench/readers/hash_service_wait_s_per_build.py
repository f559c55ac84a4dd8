"""Seconds per counted build that building threads waited for the
shared hash service's futures: growth of
``makisu_commit_stage_busy_seconds{stage="service_wait"}`` alone
(``device_wait_s_per_build`` lumps it with both readbacks). With many
builds in one process a build's wait is other builds' lanes: the
batch it rode lingered for them and read back with them."""
from pbharness import hostspans


def read(run):
    return hostspans.counter_per_build(
        run, "makisu_commit_stage_busy_seconds", stage="service_wait")
