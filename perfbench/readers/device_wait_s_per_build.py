"""Seconds per counted build that a build's thread was blocked on the
device or on the shared hash service: growth of
``makisu_commit_stage_busy_seconds`` for the stages ``gear_readback``,
``sha_readback`` and ``service_wait``. In the farm cells the SHA
readback runs on the service's thread while the builds wait in
``service_wait``, so there the sum counts that wait from both sides."""
from pbharness import hostspans

_STAGES = ("gear_readback", "sha_readback", "service_wait")


def read(run):
    waits = [hostspans.counter_per_build(
        run, "makisu_commit_stage_busy_seconds", stage=stage)
        for stage in _STAGES]
    if all(w is None for w in waits):
        return None
    return sum(w or 0.0 for w in waits)
