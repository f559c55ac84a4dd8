"""Snapshots a build that found no live session looked at, per counted
build (``makisu_session_snapshot_restores_total``, every ``result``
together: ``ok`` a session rebuilt from the snapshot plane, ``refused``
a recipe that failed a check, ``error``; a context with no recipe adds
nothing). A worker that never looked at one exports no such series:
where sessions began builds (``makisu_session_dirty_paths_total`` is
there) that is 0.0, and ``None`` only from a run without counters."""
from pbharness import hostspans


def read(run):
    looked = hostspans.counter_per_build(
        run, "makisu_session_snapshot_restores_total")
    if looked is None and hostspans.counter_per_build(
            run, "makisu_session_dirty_paths_total") is not None:
        return 0.0
    return looked
